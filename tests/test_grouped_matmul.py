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


#: id -> tokens, top_k, K, D, held, row tile: the largest calls the models
#: make (the hybrid's 4096-token bucket, a 2048-token run at widths of
#: 4096 and at DeepSeek-V3's 7168) and a prefill run that fits whole
BUDGET_SHAPES = {
    "hybrid_bucket_4096": (4096, 22, 1024, 1024, 128, 128),
    "run_2048_at_4096": (2048, 8, 4096, 4096, 16, 128),
    "run_2048_at_7168": (2048, 8, 7168, 7168, 32, 128),
    "prefill_run_2048": (2048, 8, 2048, 2048, 16, 128),
}


@pytest.mark.parametrize("shape", list(BUDGET_SHAPES))
def test_a_call_holds_at_most_what_vmem_and_smem_allow(shape):
    """`rows_a_call` gives all the rows where they fit, else the most, a
    multiple of 16, whose x, sums and tables fit the budgets; 16 more would
    not."""
    t, k, width, d, n, tm = BUDGET_SHAPES[shape]
    per_call = gm.rows_a_call(t, k, width, d, 2, n, tm)

    def fits(c):
        vmem, smem = gm._held_bytes(c, c * k, width, d, 2, n, tm)
        return vmem <= gm._ROWS_VMEM and smem <= gm._TABLE_SMEM
    assert fits(per_call)
    if shape == "prefill_run_2048":
        assert per_call == t
    else:
        assert per_call < t and per_call % 16 == 0 and not fits(per_call + 16)


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
    # room for 32 rows a call beside a tile of 64 rows (300 pairs over 16
    # experts): three runs of 32
    monkeypatch.setattr(gm, "_ROWS_VMEM",
                        gm._tile_bytes(K, D, 64) + 32 * (K + D) * 4)
    assert gm.rows_a_call(t, k, K, D, 4, 8, 64) == 32
    calls = []
    real = gm._fused_mlp
    monkeypatch.setattr(gm, "_fused_mlp", lambda x, *a, **kw: (
        calls.append(x.shape[0]), real(x, *a, **kw))[1])
    got, counts = moe.routed_experts(*args, impl="pallas",
                                     interpret=NAN_SCRATCH, init=init)
    assert calls == [32, 32, 32]
    assert list(np.asarray(counts)) == list(np.asarray(want_counts))
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)


@pytest.mark.parametrize("t,chunk", [(12, 2048), (48, 16)],
                         ids=["one_run", "three_runs_of_the_layer"])
def test_the_shared_expert_is_the_kernels_start_through_decoder_common(
        monkeypatch, t, chunk):
    """`decoder_common.moe` (kanana-2's and Trinity's expert layer) hands
    its shared SwiGLU's output to `routed_experts` as the starting value:
    the kernel receives it and its result is the layer's, the shared
    product plus the routed sum as the ragged path computes them, to 1e-5
    of the largest, with the counts exact; over several runs of the layer
    (`MOE_CHUNK`) too."""
    import functools
    import types

    from deeplearning4j_tpu.models import decoder_common as dc
    from deeplearning4j_tpu.parallel import moe
    rng = np.random.default_rng(13)
    e, held, k = 32, (8, 8), 4

    # float32: the layer's runs are jitted under `lax.map` and the oracle's
    # are not, and bfloat16 roundings would fall differently
    def mat(*shape, std=0.1):
        return jnp.asarray(rng.normal(size=shape) * std, jnp.float32)
    cfg = types.SimpleNamespace(experts_held=held, num_experts_per_tok=k,
                                routed_scaling_factor=2.5)
    layer = dict(router=mat(K, e), router_bias=jnp.asarray(
        rng.normal(size=e) * 0.01, jnp.float32),
        w_gate=mat(8, K, F), w_up=mat(8, K, F), w_down=mat(8, F, D),
        s_gate=mat(K, 256), s_up=mat(K, 256), s_down=mat(256, D))
    g = mat(t, K, std=1.0)

    starts = []
    real = gm.grouped_mlp

    def spy(*a, init=None, **kw):
        starts.append(init)
        return real(*a, init=init, **kw)
    monkeypatch.setattr(gm, "grouped_mlp", spy)
    monkeypatch.setattr(dc, "MOE_CHUNK", chunk)
    monkeypatch.setattr(dc, "routed_experts", functools.partial(
        moe.routed_experts, impl="pallas", interpret=NAN_SCRATCH))
    got, counts = dc.moe(cfg, layer, g)

    runs = max(t // chunk, 1)
    # the runs of the layer are one traced function under `lax.map`
    assert len(starts) == 1 and starts[0].shape == (t // runs, D)
    want, want_counts = [], []
    for tokens in g.reshape(runs, t // runs, K):
        routed, c = moe.routed_experts(
            tokens, dc.router_scores(layer, tokens), layer["router_bias"],
            layer["w_up"], layer["w_down"], held, k, 2.5, jax.nn.silu,
            impl="ragged", w_gate=layer["w_gate"])
        want.append(routed + dc.swiglu(tokens, layer["s_gate"],
                                       layer["s_up"], layer["s_down"])
                    .astype(jnp.float32))
        want_counts.append(c)
    want = jnp.concatenate(want)
    want_counts = jnp.stack(want_counts)
    assert got.shape == (t, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, want, rtol=0, atol=1e-5 * float(jnp.abs(want).max()))
    assert list(np.asarray(counts)) == [
        int(want_counts[:, 0].sum()), int(want_counts[:, 1].sum()),
        int(want_counts[:, 2].max())]
