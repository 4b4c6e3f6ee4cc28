"""The expert layer's grouped-matmul kernel (kernels/grouped_matmul.py) in
interpret mode against two `lax.ragged_dot`s and a weighted sum into each
row, at lane-aligned toy widths and over the group shapes that break
grouped kernels. `pltpu.InterpretParams()` fills the kernel's scratch with
NaN before it is written, so a tile's rows past its last pair, which the
kernel must neither pick nor add, would show."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels import grouped_matmul as gm
from deeplearning4j_tpu.models.nemotron_h import relu2

K, F, D = 128, 256, 128
NAN_SCRATCH = pltpu.InterpretParams()

#: id -> (group sizes, rows in the buffer), for a 16-row tile
GROUPS = {
    "empty_group_between": ([5, 0, 7], 12),
    "first_and_last_empty": ([0, 9, 4, 0], 13),
    "one_group_holds_every_row": ([0, 40, 0], 40),
    "group_larger_than_a_tile": ([3, 37, 2], 42),
    "group_ends_on_a_tile_edge": ([16, 32, 5], 53),
    "rows_past_the_last_group": ([4, 6, 3, 3], 64),
    "one_row": ([0, 1, 0], 1),
    "one_row_of_no_group": ([0, 0], 1),
    "no_row_in_any_group": ([0, 0, 0], 8),
}


def _operands(sizes, m, dtype, seed=0):
    """x (m, K), the weights, the sizes, and the rows' groups in sorted
    order: group g `sizes[g]` times, then n (no group) up to m rows."""
    rng = np.random.default_rng(seed)
    n = len(sizes)
    x = jnp.asarray(rng.normal(size=(m, K)), dtype)
    w_in = jnp.asarray(rng.normal(size=(n, K, F)) * 0.1, dtype)
    w_out = jnp.asarray(rng.normal(size=(n, F, D)) * 0.1, dtype)
    groups = np.full(m, n, np.int32)
    groups[:sum(sizes)] = np.repeat(np.arange(n), sizes)
    return x, w_in, w_out, jnp.asarray(sizes, jnp.int32), \
        jnp.asarray(groups)


def _two_ragged_dots(x, w_in, w_out, sizes, activation):
    h = lax.ragged_dot(x, w_in, sizes, preferred_element_type=jnp.float32)
    return lax.ragged_dot(activation(h).astype(x.dtype), w_out, sizes,
                          preferred_element_type=jnp.float32)


def _ragged_combined(x, w_in, w_out, groups, rows, weights, activation,
                     w_gate=None):
    """The contract the plain way: the pairs sorted by group through two
    (or, gated, three) `ragged_dot`s, each result times its weight and
    summed into its row in float32."""
    n = w_in.shape[0]
    sizes = (groups[:, None] == jnp.arange(n)[None, :]).sum(0)
    order = jnp.argsort(jnp.where((groups >= 0) & (groups < n), groups, n))
    xs = x[rows[order]]
    h = lax.ragged_dot(xs, w_in, sizes, preferred_element_type=jnp.float32)
    if w_gate is None:
        h = activation(h)
    else:
        h = activation(lax.ragged_dot(xs, w_gate, sizes,
                                      preferred_element_type=jnp.float32)) * h
    y = lax.ragged_dot(h.astype(x.dtype), w_out, sizes,
                       preferred_element_type=jnp.float32)
    held = (groups[order] >= 0) & (groups[order] < n)
    y = jnp.where(held[:, None], weights[order][:, None] * y, 0.0)
    return jnp.zeros((x.shape[0], w_out.shape[2]), jnp.float32).at[
        rows[order]].add(y)


@pytest.mark.parametrize("block_f", [None, 128], ids=["whole_f", "two_f"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_kernel_matches_two_ragged_dots(case, block_f):
    """One row a pair, weight 1: each row holds its own pair's result, and
    a row of no group is 0."""
    sizes, m = GROUPS[case]
    x, w_in, w_out, gs, groups = _operands(sizes, m, jnp.float32)
    got = gm.grouped_mlp(x, w_in, w_out, groups, relu2, block_f=block_f,
                         weights=jnp.ones((m,), jnp.float32),
                         interpret=NAN_SCRATCH)
    assert got.shape == (m, D) and got.dtype == jnp.float32
    held = sum(sizes)
    want = _two_ragged_dots(x, w_in, w_out, gs, relu2)
    # float32 both sides; the sums over K and F run in another order
    np.testing.assert_allclose(got[:held], want[:held], atol=2e-5, rtol=0)
    assert not np.asarray(got[held:]).any()


@pytest.mark.parametrize("row_tile", [16, 32, 64])
def test_row_tile_changes_the_layout_not_the_result(row_tile):
    sizes, m = [3, 37, 0, 16, 9], 96
    x, w_in, w_out, gs, groups = _operands(sizes, m, jnp.float32,
                                           seed=row_tile)
    got = gm.grouped_mlp(x, w_in, w_out, groups, jax.nn.relu,
                         weights=jnp.ones((m,), jnp.float32),
                         row_tile=row_tile, interpret=True)
    want = _two_ragged_dots(x, w_in, w_out, gs, jax.nn.relu)
    np.testing.assert_allclose(got[:65], want[:65], atol=2e-5, rtol=0)


def test_bfloat16_operands_float32_result():
    """The configuration's numerics: bfloat16 operands, float32 sums and
    activation, bfloat16 into the second product."""
    sizes, m = [4, 0, 21, 7], 48
    x, w_in, w_out, gs, groups = _operands(sizes, m, jnp.bfloat16)
    got = gm.grouped_mlp(x, w_in, w_out, groups, relu2,
                         weights=jnp.ones((m,), jnp.float32), interpret=True)
    want = _two_ragged_dots(x, w_in, w_out, gs, relu2)
    assert got.dtype == jnp.float32
    # one bfloat16 rounding of the intermediate may fall the other way
    np.testing.assert_allclose(got[:32], want[:32], atol=0.05, rtol=0.02)
    exact = _two_ragged_dots(*(a.astype(jnp.float32)
                               for a in (x, w_in, w_out)), gs, relu2)
    assert float(jnp.abs(got[:32] - exact[:32]).max()) \
        < 2 * float(jnp.abs(want[:32] - exact[:32]).max()) + 1e-3


def _start(t, seed):
    """A starting value for t rows' sums: a shared expert's output, float32
    of about the size the routed results have"""
    return jnp.asarray(np.random.default_rng(seed).normal(size=(t, D)),
                       jnp.float32)


def _pairs_in_any_order(dtype, start):
    """11 tokens, 40 pairs shuffled over four groups (one empty) and no
    group, several pairs a row: (the kernel's sums, the ragged path's, the
    operands)."""
    sizes, m = [6, 0, 19, 2], 40
    x, w_in, w_out, gs, groups = _operands(sizes, m, dtype, seed=3)
    rng = np.random.default_rng(4)
    shuffle = rng.permutation(m)
    rows = jnp.asarray(rng.integers(0, 11, m), jnp.int32)     # 11 tokens
    weights = jnp.asarray(rng.uniform(0.1, 1.0, m), jnp.float32)
    init = _start(11, 5) if start else None
    got = gm.grouped_mlp(x[:11], w_in, w_out, groups[shuffle], jax.nn.relu,
                         rows=rows, weights=weights, interpret=NAN_SCRATCH,
                         init=init)
    assert got.shape == (11, D) and got.dtype == jnp.float32
    want = _ragged_combined(x[:11], w_in, w_out, groups[shuffle], rows,
                            weights, jax.nn.relu)
    if start:
        want = init + want
    return got, want, (x, w_in, w_out, groups[shuffle], rows, weights)


def test_pairs_in_any_order_reading_any_row():
    """The pairs need not come sorted, and `rows` lets several read one row
    of x and add into it (a token's top-k choices): the kernel's layout
    does the sorting, and every row gets the weighted sum of its own
    pairs' results, each pair's product checked one by one."""
    got, want, (x, w_in, w_out, groups, rows, weights) = \
        _pairs_in_any_order(jnp.float32, start=False)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)
    want = np.zeros((11, D), np.float32)
    for i in range(groups.shape[0]):
        g = int(groups[i])
        if g < w_in.shape[0]:
            want[int(rows[i])] += float(weights[i]) * np.asarray(
                jax.nn.relu(x[rows[i]] @ w_in[g]) @ w_out[g])
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)


@pytest.mark.parametrize("start", [False, True], ids=["zero", "from_a_start"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_pairs_in_any_order_in_either_dtype_from_either_start(dtype, start):
    """The same pairs in float32 and bfloat16 rows, the sums started at 0 or
    at a starting value: the ragged path's sums (plus the start) to 1e-5
    of the largest. Eleven rows: bfloat16 rows go in two to a 32-bit word,
    the last word half padding, and a row of either half is picked
    exactly; the sum has 16 rows, the start's last five padding."""
    got, want, _ = _pairs_in_any_order(dtype, start)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=1e-5 * float(jnp.abs(want).max()))


def test_bfloat16_rows_go_in_two_to_a_word_and_come_out_whole():
    """`_words` keeps every bit of a bfloat16 row: row 2j in the low half of
    word row j, row 2j + 1 in the high; the kernel's pick, a shift or a
    mask, gives the row's float32 back."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(7, 256)),
                    jnp.bfloat16)
    words = gm._words(x)
    assert words.shape == (4, 256) and words.dtype == jnp.uint32
    low = lax.bitcast_convert_type(words << 16, jnp.float32)
    high = lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                    jnp.float32)
    back = jnp.stack([low, high], 1).reshape(8, 256)
    np.testing.assert_array_equal(back[:7], x.astype(jnp.float32))
    assert not np.asarray(back[7]).any()
    assert gm._words(x.astype(jnp.float32)).dtype == jnp.float32
    with pytest.raises(ValueError, match="float32 or bfloat16"):
        gm._words(x.astype(jnp.float16))


def _top_k_pairs(t, e, k, held, seed):
    """A router's choice at toy size: each of t tokens picks k of e experts,
    this chip holds experts 0..held-1; the pairs token-major as
    `routed_experts` lays them, a pair of an expert held elsewhere in no
    group."""
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.normal(size=(t, e)), axis=-1)[:, :k]
    groups = np.where(idx < held, idx, held).reshape(-1)
    rows = np.arange(t * k) // k
    weights = rng.uniform(0.0, 1.0, (t, k))
    weights /= weights.sum(-1, keepdims=True)
    return (jnp.asarray(groups, jnp.int32), jnp.asarray(rows, jnp.int32),
            jnp.asarray(weights.reshape(-1), jnp.float32))


@pytest.mark.parametrize("start", [False, True], ids=["zero", "from_a_start"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("block_f", [None, 128], ids=["whole_f", "two_f"])
def test_combined_rows_match_the_ragged_path_with_most_pairs_elsewhere(
        block_f, dtype, start):
    """A long prefill's shape at toy widths: 48 tokens pick 8 of 128
    experts and this chip holds 16, so about seven pairs in eight belong
    to no group. Gated, as the long-context models' experts are; the sums
    start at 0 or at a shared expert's output."""
    t, held = 48, 16
    groups, rows, weights = _top_k_pairs(t, 128, 8, held, seed=7)
    assert int((groups < held).sum()) < t * 8 // 4
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(t, K)), dtype)
    w_gate, w_in = (jnp.asarray(rng.normal(size=(held, K, F)) * 0.1, dtype)
                    for _ in range(2))
    w_out = jnp.asarray(rng.normal(size=(held, F, D)) * 0.1, dtype)
    init = _start(t, 9) * 0.01 if start else None
    got = gm.grouped_mlp(x, w_in, w_out, groups, jax.nn.silu, rows=rows,
                         weights=weights, block_f=block_f, w_gate=w_gate,
                         interpret=NAN_SCRATCH, init=init)
    want = _ragged_combined(x, w_in, w_out, groups, rows, weights,
                            jax.nn.silu, w_gate)
    if start:
        want = init + want
    assert got.shape == (t, D) and got.dtype == jnp.float32
    # the same products; only the order of each row's float32 sum differs
    atol = 2e-5 if dtype == jnp.float32 else 1e-5 * float(
        jnp.abs(want).max()) + 1e-6
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)


def test_a_call_where_no_pair_has_a_group_is_zero():
    """Every chosen expert held elsewhere: the grid visits tile 0 once,
    picks no row, adds nothing; the result is 0, not the scratch's NaN."""
    t = 12
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(t, K)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(4, K, F)), jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(4, F, D)), jnp.float32)
    groups = jnp.full((t * 2,), 4, jnp.int32)
    got = gm.grouped_mlp(x, w_in, w_out, groups, jax.nn.relu,
                         rows=jnp.arange(t * 2, dtype=jnp.int32) // 2,
                         weights=jnp.ones((t * 2,), jnp.float32),
                         interpret=NAN_SCRATCH)
    assert got.shape == (t, D)
    assert not np.asarray(got).any()


def test_a_call_where_no_pair_has_a_group_is_its_start():
    """The same call handed a starting value: the result is the start, bit
    for bit, though its 12 rows are padded to the sum's 16 in the kernel."""
    t = 12
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(t, K)), jnp.bfloat16)
    w_in = jnp.asarray(rng.normal(size=(4, K, F)), jnp.bfloat16)
    w_out = jnp.asarray(rng.normal(size=(4, F, D)), jnp.bfloat16)
    init = _start(t, 10)
    got = gm.grouped_mlp(x, w_in, w_out, jnp.full((t * 2,), 4, jnp.int32),
                         jax.nn.relu,
                         rows=jnp.arange(t * 2, dtype=jnp.int32) // 2,
                         weights=jnp.ones((t * 2,), jnp.float32),
                         interpret=NAN_SCRATCH, init=init)
    np.testing.assert_array_equal(got, init)


def test_layout_puts_every_group_on_a_tile_and_visits_no_empty_one():
    sizes = [3, 0, 17, 16, 0, 1]
    groups = np.full(64, 6, np.int32)
    groups[:37] = np.repeat(np.arange(6), sizes)
    shuffle = np.random.default_rng(0).permutation(64)
    tile_group, tile_count, tiles, tile_pair = gm._layout(
        jnp.asarray(groups[shuffle]), 6, 16)
    assert int(tiles) == 1 + 2 + 1 + 1
    assert tile_group.shape == (64 // 16 + 6,)
    assert list(tile_group[:5]) == [0, 2, 2, 3, 5]
    # a group's last tile holds its remainder from its first row on
    assert list(tile_count) == [3, 16, 1, 16, 1] + [0] * 5
    tile_pair = np.asarray(tile_pair).reshape(-1, 16)
    placed = [(v, i, int(tile_pair[v, i])) for v in range(10)
              for i in range(int(tile_count[v]))]
    # every pair of a group sits in exactly one used row, of its group's tile
    held = np.flatnonzero(groups[shuffle] < 6)
    assert sorted(p for _, _, p in placed) == list(held)
    for v, _, p in placed:
        assert tile_group[v] == groups[shuffle[p]]


def test_widths_mosaic_cannot_take_are_named():
    assert gm.supported(1024, 2688, 1024)
    assert not gm.supported(32, 84, 32)           # the CPU tests' toy widths
    assert not gm.supported(128, 84, 128)
    # the published expert's blocks whole: 22 MB of VMEM, double-buffered
    assert gm._block_f(1024, 2688, 1024, 2) == 2688
    assert gm._block_f(8192, 2688, 8192, 2) == 384


@pytest.mark.parametrize("at", [0, 16, 32])
def test_a_call_at_a_row_of_a_buffer_writes_its_rows_and_no_other(at):
    """`at`: a call's 16 rows of a (48, D) float32 buffer start its sums
    and take them, bit for bit what the call makes from those rows alone;
    the buffer's other rows come back as they were; a buffer that does not
    hold the rows, or is not float32, is refused."""
    x, w_in, w_out, _, groups = _operands([5, 0, 9], 16, jnp.bfloat16,
                                          seed=12)
    buffer = _start(48, 13)
    weights = jnp.asarray(np.random.default_rng(14).uniform(size=16),
                          jnp.float32)

    def call(init, at=None):
        return gm.grouped_mlp(x, w_in, w_out, groups, jax.nn.relu,
                              weights=weights, interpret=NAN_SCRATCH,
                              init=init, at=at)
    got = call(buffer, at)
    assert got.shape == (48, D)
    np.testing.assert_array_equal(got[at:at + 16], call(buffer[at:at + 16]))
    mask = np.ones(48, bool)
    mask[at:at + 16] = False
    np.testing.assert_array_equal(np.asarray(got)[mask],
                                  np.asarray(buffer)[mask])
    with pytest.raises(ValueError, match="buffer"):
        call(buffer, 40)
    with pytest.raises(ValueError, match="buffer"):
        call(buffer.astype(jnp.bfloat16), at)


#: id -> tokens, top_k, K, D, held, row tile, expert width, gated: the
#: largest calls the models make (the hybrid's 4096-token bucket, a
#: 2048-token run at widths of 4096 and at DeepSeek-V3's 7168) and a
#: prefill run that fits whole
BUDGET_SHAPES = {
    "hybrid_bucket_4096": (4096, 22, 1024, 1024, 128, 128, 2688, False),
    "run_2048_at_4096": (2048, 8, 4096, 4096, 16, 128, 1024, True),
    "run_2048_at_7168": (2048, 8, 7168, 7168, 32, 128, 2048, True),
    "prefill_run_2048": (2048, 8, 2048, 2048, 16, 128, 768, True),
}


@pytest.mark.parametrize("shape", list(BUDGET_SHAPES))
def test_a_call_holds_at_most_what_vmem_and_smem_allow(shape):
    """`rows_a_call` gives all the rows where they fit, else the most, a
    multiple of 16, whose x, sums and tables fit the budgets (the rows'
    VMEM what the call's weight blocks leave of `_CALL_VMEM`); 16 more
    would not."""
    t, k, width, d, n, tm, f, gated = BUDGET_SHAPES[shape]
    blocks = gm.weight_bytes((2 if gated else 1) * width, f, d, 2)
    per_call = gm.rows_a_call(t, k, width, d, 2, n, tm, blocks)

    def fits(c):
        vmem, smem = gm._held_bytes(c, c * k, width, d, 2, n, tm)
        return vmem <= gm._CALL_VMEM - blocks and smem <= gm._TABLE_SMEM
    assert fits(per_call)
    if shape == "prefill_run_2048":
        assert per_call == t
    else:
        assert per_call < t and per_call % 16 == 0 and not fits(per_call + 16)


def test_the_rows_take_what_the_weight_blocks_leave():
    """Narrower weight blocks leave the rows more of one call's VMEM, and
    the widest blocks (`_WEIGHT_VMEM`) still leave them 40 MiB: a call
    that fit beside fixed budgets of 48 and 40 MiB fits as it was."""
    assert gm._CALL_VMEM - gm._WEIGHT_VMEM == 40 << 20
    lfm2 = gm.weight_bytes(2 * 2048, 1536, 2048, 2)      # 36 MiB
    keye = gm.weight_bytes(2 * 2048, 768, 2048, 2)       # 18 MiB
    assert lfm2 == 36 << 20 and keye == 18 << 20
    calls = [gm.rows_a_call(16384, 4, 2048, 2048, 2, 64, 128, blocks)
             for blocks in (gm._WEIGHT_VMEM, lfm2, keye)]
    assert calls == sorted(calls) and calls[0] < 4096 <= calls[1]


def test_a_call_over_the_budget_is_refused():
    x = jnp.zeros((4096, 4096), jnp.bfloat16)
    w_in = jnp.zeros((2, 4096, 128), jnp.bfloat16)
    w_out = jnp.zeros((2, 128, 4096), jnp.bfloat16)
    groups = jnp.zeros((4096,), jnp.int32)
    with pytest.raises(ValueError, match="rows_a_call"):
        jax.eval_shape(lambda x: gm.grouped_mlp(
            x, w_in, w_out, groups, weights=jnp.ones((4096,))), x)


@pytest.mark.parametrize("start", [False, True], ids=["zero", "from_a_start"])
def test_tokens_past_one_call_go_in_runs(monkeypatch, start):
    """Where the tokens do not fit one call, `routed_experts` takes them in
    runs of equal length, the last padded with tokens of no pair, each run
    its rows of the starting value where one is given: the result and the
    counts are the ragged path's."""
    from deeplearning4j_tpu.parallel import moe
    rng = np.random.default_rng(11)
    t, e, k = 75, 16, 4
    x = jnp.asarray(rng.normal(size=(t, K)), jnp.float32)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(t, e)),
                                        jnp.float32))
    w_in = jnp.asarray(rng.normal(size=(8, K, F)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(8, F, D)) * 0.1, jnp.float32)
    args = (x, scores, None, w_in, w_out, (4, 8), k, 2.0, jax.nn.relu)
    init = _start(t, 12) if start else None
    want, want_counts = moe.routed_experts(*args, impl="ragged", init=init)
    # room for 32 rows a call beside the weight blocks and a tile of 64
    # rows (300 pairs over 16 experts): three runs of 32
    blocks = gm.weight_bytes(K, F, D, 4)
    monkeypatch.setattr(gm, "_CALL_VMEM", blocks + gm._tile_bytes(K, D, 64)
                        + 32 * (K + D) * 4)
    assert gm.rows_a_call(t, k, K, D, 4, 8, 64, blocks) == 32
    calls = []
    real = gm._fused_mlp
    monkeypatch.setattr(gm, "_fused_mlp", lambda x, *a, **kw: (
        calls.append(x.shape[0]), real(x, *a, **kw))[1])
    got, counts = moe.routed_experts(*args, impl="pallas",
                                     interpret=NAN_SCRATCH, init=init)
    assert calls == [32, 32, 32]
    assert list(np.asarray(counts)) == list(np.asarray(want_counts))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)


def _layer(shared, dtype=jnp.float32, seed=13):
    """A sigmoid-routed expert layer at lane-aligned toy widths: 32
    experts, 4 a token, 8 held here (16-23); with a shared SwiGLU of 256
    (Trinity's and kanana-2's kind) or without one (LFM2's)."""
    import types
    rng = np.random.default_rng(seed)

    def mat(*shape, std=0.1):
        return jnp.asarray(rng.normal(size=shape) * std, dtype)
    cfg = types.SimpleNamespace(experts_held=(16, 8), num_experts_per_tok=4,
                                routed_scaling_factor=2.5,
                                compute_dtype=dtype)
    layer = dict(router=mat(K, 32), router_bias=jnp.asarray(
        rng.normal(size=32) * 0.01, jnp.float32),
        w_gate=mat(8, K, F), w_up=mat(8, K, F), w_down=mat(8, F, D))
    if shared:
        layer.update(s_gate=mat(K, 256), s_up=mat(K, 256),
                     s_down=mat(256, D))
    return cfg, layer


def _on_the_kernel_path(monkeypatch, per_call=None, itemsize=4, tm=16):
    """`decoder_common.moe` steered as a TPU steers it (the kernel,
    interpreted here) with room for `per_call` tokens of `itemsize` bytes
    a call of the kernel at row tile `tm` (a prompt that fits one call
    where None), a multiple of 16; returns the starting values the kernel
    is handed and the rows of them each call takes (`at`), in the order
    of its calls."""
    import functools

    from deeplearning4j_tpu.models import decoder_common as dc
    from deeplearning4j_tpu.parallel import moe
    starts = []
    real = gm.grouped_mlp

    def spy(*a, init=None, at=None, **kw):
        starts.append((init, at))
        return real(*a, init=init, at=at, **kw)
    monkeypatch.setattr(gm, "grouped_mlp", spy)
    monkeypatch.setattr(dc, "takes_kernel", lambda w_in, w_out: True)
    monkeypatch.setattr(dc, "routed_experts", functools.partial(
        moe.routed_experts, impl="pallas", interpret=NAN_SCRATCH))
    if per_call is not None:
        # gated blocks of K x F
        monkeypatch.setattr(gm, "_CALL_VMEM", gm.weight_bytes(
            2 * K, F, D, itemsize) + gm._tile_bytes(K, D, tm)
            + per_call * (K * itemsize + D * 4))
    return starts


def _ragged_layer(cfg, layer, g):
    """The expert layer the ragged way, whole: (out, counts)."""
    from deeplearning4j_tpu.models import decoder_common as dc
    from deeplearning4j_tpu.parallel import moe
    routed, counts = moe.routed_experts(
        g, dc.router_scores(layer, g), layer["router_bias"], layer["w_up"],
        layer["w_down"], cfg.experts_held, cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, jax.nn.silu, impl="ragged",
        w_gate=layer["w_gate"])
    if "s_gate" in layer:
        routed = routed + dc.swiglu(g, layer["s_gate"], layer["s_up"],
                                    layer["s_down"]).astype(jnp.float32)
    return routed, counts


@pytest.mark.parametrize("t,chunk", [(12, 2048), (48, 16)],
                         ids=["one_run", "three_runs_of_the_layer"])
def test_the_shared_expert_is_the_kernels_start_through_decoder_common(
        monkeypatch, t, chunk):
    """`decoder_common.moe` (kanana-2's and Trinity's expert layer) hands
    its shared SwiGLU's output to `routed_experts` as the starting value:
    the kernel receives it and its result is the layer's, the shared
    product plus the routed sum as the ragged path computes them, to 1e-5
    of the largest, with the counts exact; over several calls of the
    kernel too, where the prompt is longer than one call holds (`chunk`
    tokens a call): the layer takes the whole prompt and each call of the
    kernel starts from its run's rows of the one buffer the calls pass on,
    and writes its sums over them."""
    from deeplearning4j_tpu.models import decoder_common as dc

    # float32: bfloat16 roundings would fall differently in the oracle
    cfg, layer = _layer(shared=True)
    g = jnp.asarray(np.random.default_rng(14).normal(size=(t, K)),
                    jnp.float32)
    starts = _on_the_kernel_path(monkeypatch,
                                 per_call=chunk if chunk < t else None)
    got, counts = dc.moe(cfg, layer, g)

    if chunk >= t:
        assert [(s.shape, at) for s, at in starts] == [((t, D), None)]
    else:
        assert [(s.shape, at) for s, at in starts] \
            == [((t, D), at) for at in range(0, t, chunk)]
    want, want_counts = _ragged_layer(cfg, layer, g)
    assert got.shape == (t, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, want, rtol=0, atol=1e-5 * float(jnp.abs(want).max()))
    assert list(np.asarray(counts)) == list(np.asarray(want_counts))


def test_a_layer_without_a_shared_expert_past_one_call_is_the_ragged_one(
        monkeypatch):
    """LFM2's kind of layer (no shared expert: each run's sum starts at
    zero) over a prompt of three calls of the kernel: the ragged path's
    outputs to 1e-5 of the largest and its counts exactly."""
    from deeplearning4j_tpu.models import decoder_common as dc
    cfg, layer = _layer(shared=False)
    g = jnp.asarray(np.random.default_rng(15).normal(size=(48, K)),
                    jnp.float32)
    starts = _on_the_kernel_path(monkeypatch, per_call=16)
    got, counts = dc.moe(cfg, layer, g)
    assert starts == [(None, None)] * 3
    want, want_counts = _ragged_layer(cfg, layer, g)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=1e-5 * float(jnp.abs(want).max()))
    assert list(np.asarray(counts)) == list(np.asarray(want_counts))


@pytest.mark.parametrize("shared", [False, True],
                         ids=["no_shared_expert", "shared_start"])
def test_a_tokens_result_is_the_same_bits_in_runs_of_any_length(
        monkeypatch, shared):
    """The same bfloat16 layer over 96 tokens in one call of the kernel
    and in runs of 16, 32 and 48 (6, 3 and 2 calls): a token's pairs all
    fall in one call and are summed in the same order after the same
    start, so its result is the same to the bit, and so are the counts."""
    from deeplearning4j_tpu.models import decoder_common as dc
    cfg, layer = _layer(shared, jnp.bfloat16)
    g = jnp.asarray(np.random.default_rng(16).normal(size=(96, K)),
                    jnp.bfloat16)
    got = {}
    for per_call in (None, 16, 32, 48):
        with monkeypatch.context() as m:
            # 12 pairs an expert on average: a row tile of 32
            starts = _on_the_kernel_path(m, per_call, itemsize=2, tm=32)
            out, counts = dc.moe(cfg, layer, g)
        assert len(starts) == (1 if per_call is None else 96 // per_call)
        got[per_call] = (np.asarray(out), list(np.asarray(counts)))
    whole, whole_counts = got.pop(None)
    for per_call, (out, counts) in got.items():
        np.testing.assert_array_equal(out, whole, err_msg=str(per_call))
        assert counts == whole_counts


#: a serving cell's configuration -> (its family's module, its decoder,
#: its slots, tokens a call of `routed_experts` takes in an admit of 8192
#: and of 16384, and how many times one expert layer of such an admit
#: reads its experts on a TPU and on the CPU). Keye cuts a prompt into
#: runs of `MOE_CHUNK` itself (`keye_vl.moe`), on either path; the hybrid
#: (`nemotron_h.moe_mixer`) hands `routed_experts` the whole prompt on
#: either, and its tables in SMEM hold 2048-2352 tokens of 22 pairs a
#: call; the rest hand the kernel the whole prompt, and take runs of
#: `MOE_CHUNK` on the ragged path
_WHOLE, _CHUNKS = {8192: 8192, 16384: 16384}, {8192: 4, 16384: 8}
PUBLISHED = {
    "lfm2_24b_a2b_pp5": ("lfm2_moe", "Lfm2MoeDecoder", 48, _WHOLE,
                         {8192: 2, 16384: 4}, _CHUNKS),
    "trinity_mini_ep8": ("afmoe", "AfmoeDecoder", 48, _WHOLE,
                         {8192: 2, 16384: 4}, _CHUNKS),
    "kanana2_30b_a3b_ep8": ("deepseek_v3", "MLADecoder", 48, _WHOLE,
                            {8192: 2, 16384: 3}, _CHUNKS),
    "keye_vl2_30b_a3b_ep8": ("keye_vl", "KeyeDecoder", 32,
                             {8192: 2048, 16384: 2048}, _CHUNKS, _CHUNKS),
    "nemotron3_super_120b_ep4": ("nemotron_h", "NemotronHDecoder", 128,
                                 _WHOLE, {8192: 4, 16384: 7},
                                 {8192: 1, 16384: 1}),
}


@pytest.mark.parametrize("config", list(PUBLISHED))
def test_the_published_expert_layers_take_these_runs(monkeypatch, config):
    """At the published widths, as a TPU runs them: how many times one
    expert layer of an admit of 8192 and of 16384 tokens reads its experts
    (the decoder's `expert_runs`, which `GenerationServer.status()` shows),
    once a decode step of the cell's slots; the tokens of each call, and
    every call's scoped VMEM 24 MiB or more under a v5e core's 128 MiB;
    and how many on the CPU, where the ragged path takes the layer."""
    import importlib
    import json
    import os

    from deeplearning4j_tpu.generation import decode
    from deeplearning4j_tpu.models import decoder_common
    from deeplearning4j_tpu.parallel import moe
    module, decoder, slots, tokens, runs, on_cpu = PUBLISHED[config]
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmarks", "configs",
                           config + ".json")) as f:
        file = json.load(f)
    family = importlib.import_module(
        f"benchmarks.families.{file['family']}")
    model = importlib.import_module(f"deeplearning4j_tpu.models.{module}")
    cfg = family.model_config(file, "bfloat16")
    params = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dec = getattr(decode, decoder)(cfg, params)
    assert {b: dec.expert_runs(b) for b in runs} == on_cpu

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert {b: dec.expert_runs(b) for b in runs} == runs
    assert dec.expert_runs(slots) == 1
    layer = decoder_common.expert_layer(params)
    w_in, w_out = (layer["w_up"], layer["w_down"]) if "w_up" in layer \
        else (layer["w1"], layer["w2"])
    w_gate = layer.get("w_gate")
    n, width, f = w_in.shape
    k = cfg.num_experts_per_tok
    for b, t in [*tokens.items(), (slots, slots)]:
        tm = moe._row_tile(t * k / layer["router"].shape[1])
        calls, run = moe.kernel_runs(t, k, tm, jnp.bfloat16, w_in, w_out,
                                     w_gate)
        assert calls * (b // t) == dec.expert_runs(b)
        assert run % 16 == 0 or calls == 1
        vmem, _ = gm._held_bytes(run, run * k, width, width, 2, n, tm)
        blocks = gm.weight_bytes((1 if w_gate is None else 2) * width, f,
                                 width, 2)
        assert gm.vmem_limit(blocks, vmem) <= 104 << 20, (b, run)
