"""The expert layer's grouped-matmul kernel (kernels/grouped_matmul.py) in
interpret mode against two `lax.ragged_dot`s, at lane-aligned toy widths
and over the group shapes that break grouped kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.kernels import grouped_matmul as gm
from deeplearning4j_tpu.models.nemotron_h import relu2

K, F, D = 128, 256, 128

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


@pytest.mark.parametrize("block_f", [None, 128], ids=["whole_f", "two_f"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_kernel_matches_two_ragged_dots(case, block_f):
    sizes, m = GROUPS[case]
    x, w_in, w_out, gs, groups = _operands(sizes, m, jnp.float32)
    got = gm.grouped_mlp(x, w_in, w_out, groups, relu2, block_f=block_f,
                         interpret=True)
    assert got.shape == (m, D) and got.dtype == jnp.float32
    held = sum(sizes)
    want = _two_ragged_dots(x, w_in, w_out, gs, relu2)
    # float32 both sides; the sums over K and F run in another order
    np.testing.assert_allclose(got[:held], want[:held], atol=2e-5, rtol=0)


@pytest.mark.parametrize("row_tile", [16, 32, 64])
def test_row_tile_changes_the_layout_not_the_result(row_tile):
    sizes, m = [3, 37, 0, 16, 9], 96
    x, w_in, w_out, gs, groups = _operands(sizes, m, jnp.float32,
                                           seed=row_tile)
    got = gm.grouped_mlp(x, w_in, w_out, groups, jax.nn.relu,
                         row_tile=row_tile, interpret=True)
    want = _two_ragged_dots(x, w_in, w_out, gs, jax.nn.relu)
    np.testing.assert_allclose(got[:65], want[:65], atol=2e-5, rtol=0)


def test_bfloat16_operands_float32_result():
    """The configuration's numerics: bfloat16 operands, float32 sums and
    activation, bfloat16 into the second product."""
    sizes, m = [4, 0, 21, 7], 48
    x, w_in, w_out, gs, groups = _operands(sizes, m, jnp.bfloat16)
    got = gm.grouped_mlp(x, w_in, w_out, groups, relu2, interpret=True)
    want = _two_ragged_dots(x, w_in, w_out, gs, relu2)
    assert got.dtype == jnp.float32
    # one bfloat16 rounding of the intermediate may fall the other way
    np.testing.assert_allclose(got[:32], want[:32], atol=0.05, rtol=0.02)
    exact = _two_ragged_dots(*(a.astype(jnp.float32)
                               for a in (x, w_in, w_out)), gs, relu2)
    assert float(jnp.abs(got[:32] - exact[:32]).max()) \
        < 2 * float(jnp.abs(want[:32] - exact[:32]).max()) + 1e-3


def test_pairs_in_any_order_reading_any_row():
    """The pairs need not come sorted, and `rows` lets several read one row
    of x (a token's top-k choices): the kernel's layout does the sorting,
    and every pair gets its own result back in its own place."""
    sizes, m = [6, 0, 19, 2], 40
    x, w_in, w_out, gs, groups = _operands(sizes, m, jnp.float32, seed=3)
    rng = np.random.default_rng(4)
    shuffle = rng.permutation(m)
    rows = jnp.asarray(rng.integers(0, 10, m), jnp.int32)     # 10 tokens
    got = gm.grouped_mlp(x[:10], w_in, w_out, groups[shuffle], jax.nn.relu,
                         rows=rows, interpret=True)
    for i in range(m):
        g = int(groups[shuffle[i]])
        if g < len(sizes):
            want = jax.nn.relu(x[rows[i]] @ w_in[g]) @ w_out[g]
            np.testing.assert_allclose(got[i], want, atol=2e-5, rtol=0)


def test_layout_puts_every_group_on_a_tile_and_visits_no_empty_one():
    sizes = [3, 0, 17, 16, 0, 1]
    groups = np.full(64, 6, np.int32)
    groups[:37] = np.repeat(np.arange(6), sizes)
    shuffle = np.random.default_rng(0).permutation(64)
    rows = jnp.arange(64, dtype=jnp.int32)
    tile_group, tiles, tile_rows, tiled = gm._layout(
        jnp.asarray(groups[shuffle]), rows, 6, 16)
    assert int(tiles) == 1 + 2 + 1 + 1
    assert tile_group.shape == (64 // 16 + 6,)
    assert list(tile_group[:5]) == [0, 2, 2, 3, 5]
    tiled, tile_rows = np.asarray(tiled), np.asarray(tile_rows)
    held = groups[shuffle] < 6
    # every pair of a group has a tiled row of its own that reads its row
    assert list(tile_rows[tiled[held]]) == list(np.arange(64)[held])
    assert len(set(tiled[held].tolist())) == 37
    # in its group's tiles: groups start at tiled rows 0, 16, 48, 64
    starts = {0: 0, 2: 16, 3: 48, 5: 64}
    for i in np.flatnonzero(held):
        g = groups[shuffle[i]]
        assert starts[g] <= tiled[i] < starts[g] + sizes[g]
    assert tile_rows.max() < 64 and tile_rows.min() >= 0
    assert not tiled[~held].any()


def test_widths_mosaic_cannot_take_are_named():
    assert gm.supported(1024, 2688, 1024)
    assert not gm.supported(32, 84, 32)           # the CPU tests' toy widths
    assert not gm.supported(128, 84, 128)
    # the published expert's blocks whole: 22 MB of VMEM, double-buffered
    assert gm._block_f(1024, 2688, 1024, 2) == 2688
    assert gm._block_f(8192, 2688, 8192, 2) == 384
